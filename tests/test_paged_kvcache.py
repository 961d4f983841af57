"""Paged KV-cache subsystem: allocator invariants, and exact (bitwise)
equivalence of the paged append/read path against the dense cache for
every KV format across ragged per-slot positions — paging must be a pure
layout change."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvcache as KV
from repro.core import paged_kvcache as PKV
from repro.core.precision import get_policy


def _spec(fmt):
    return get_policy(f"w16a16{fmt}").kv


# ---------------------------------------------------------------------------
# BlockAllocator invariants
# ---------------------------------------------------------------------------


class TestAllocator:
    def test_no_double_alloc(self):
        a = PKV.BlockAllocator(16)
        seen = set()
        for _ in range(4):
            blks = a.alloc(4)
            assert not (seen & set(blks))          # disjoint from all prior
            assert len(set(blks)) == len(blks)     # and internally
            seen |= set(blks)
        assert seen == set(range(16))

    def test_oom_raises(self):
        a = PKV.BlockAllocator(4)
        a.alloc(3)
        with pytest.raises(PKV.OutOfBlocksError):
            a.alloc(2)
        assert a.free_count == 1                   # failed alloc took nothing
        a.alloc(1)

    def test_free_returns_blocks(self):
        a = PKV.BlockAllocator(8)
        blks = a.alloc(5)
        assert a.free_count == 3
        a.free(blks[:2])
        assert a.free_count == 5
        again = a.alloc(5)
        assert len(set(again)) == 5
        assert not (set(again) & set(blks[2:]))    # still-held stay held

    def test_double_free_rejected(self):
        a = PKV.BlockAllocator(4)
        blks = a.alloc(2)
        a.free(blks)
        with pytest.raises(ValueError):
            a.free([blks[0]])

    def test_foreign_free_rejected(self):
        a = PKV.BlockAllocator(4)
        held = a.alloc(1)
        never_allocated = next(b for b in range(4) if b not in held)
        with pytest.raises(ValueError):
            a.free([never_allocated])

    def test_reset(self):
        a = PKV.BlockAllocator(6)
        a.alloc(6)
        a.reset()
        assert a.free_count == 6 and a.can_alloc(6)

    def test_can_alloc(self):
        a = PKV.BlockAllocator(3)
        assert a.can_alloc(3) and not a.can_alloc(4)
        a.alloc(2)
        assert a.can_alloc(1) and not a.can_alloc(2)

    def test_blocks_needed(self):
        assert PKV.blocks_needed(1, 8) == 1
        assert PKV.blocks_needed(8, 8) == 1
        assert PKV.blocks_needed(9, 8) == 2
        assert PKV.blocks_needed(0, 8) == 1        # floor of one block


# ---------------------------------------------------------------------------
# Refcounted sharing + prefix-cache retention (DESIGN.md §5.2 lifecycle)
# ---------------------------------------------------------------------------


class TestRefcounting:
    def test_share_release_interleavings(self):
        """A block frees only at refcount 0, whatever the interleaving."""
        a = PKV.BlockAllocator(4)
        [b] = a.alloc(1)
        a.share(b)                      # rc 2
        a.share(b)                      # rc 3
        assert a.refcount(b) == 3
        a.free([b])                     # rc 2 — still live
        assert a.refcount(b) == 2 and a.free_count == 3
        a.share(b)                      # rc 3 again after a partial release
        a.free([b, b])                  # rc 1
        assert a.refcount(b) == 1 and a.free_count == 3
        a.free([b])                     # rc 0 → FREE
        assert a.refcount(b) == 0 and a.free_count == 4
        with pytest.raises(ValueError):
            a.free([b])                 # double free still rejected

    def test_share_of_free_block_rejected(self):
        a = PKV.BlockAllocator(2)
        held = a.alloc(1)
        free_block = next(b for b in range(2) if b not in held)
        with pytest.raises(ValueError):
            a.share(free_block)

    def test_shared_alloc_accounting(self):
        """Sharing takes no new blocks: OutOfBlocks triggers on physical
        blocks, not references."""
        a = PKV.BlockAllocator(4)
        blks = a.alloc(3)
        for b in blks:
            a.share(b)                  # 6 references, 3 physical blocks
        assert a.free_count == 1 and a.can_alloc(1)
        a.alloc(1)
        with pytest.raises(PKV.OutOfBlocksError):
            a.alloc(1)
        # releasing one reference per shared block frees nothing yet
        a.free(blks)
        assert a.free_count == 0 and not a.can_alloc(1)
        a.free(blks)
        assert a.free_count == 3

    def test_cacheable_parks_on_lru_and_revives(self):
        a = PKV.BlockAllocator(4)
        [b] = a.alloc(1)
        a.set_cacheable(b)
        a.free([b])
        assert a.refcount(b) == 0
        assert a.cached_count == 1 and a.free_count == 3
        assert a.available == 4         # cached blocks still allocatable
        a.share(b)                      # prefix hit: revive to LIVE
        assert a.refcount(b) == 1 and a.cached_count == 0

    def test_lru_eviction_order_and_callback(self):
        """alloc evicts refcount-0 cached blocks oldest-first, notifying
        on_evict, and never before the free list is exhausted."""
        evicted = []
        a = PKV.BlockAllocator(3, on_evict=evicted.append)
        b0, b1, b2 = a.alloc(3)
        for b in (b0, b1, b2):
            a.set_cacheable(b)
        a.free([b1])                    # LRU order: b1 (oldest), then b2
        a.free([b2])
        got = a.alloc(2)
        assert evicted == [b1, b2]      # oldest-first
        assert set(got) == {b1, b2}
        assert a.refcount(b0) == 1      # live block untouched

    def test_eviction_never_touches_live_blocks(self):
        a = PKV.BlockAllocator(3, on_evict=lambda b: None)
        live = a.alloc(2)
        [c] = a.alloc(1)
        a.set_cacheable(c)
        a.free([c])                     # 0 free, 1 cached, 2 live
        a.alloc(1)                      # must evict c, not a live block
        for b in live:
            assert a.refcount(b) == 1
        with pytest.raises(PKV.OutOfBlocksError):
            a.alloc(1)                  # only live blocks remain

    def test_set_cacheable_requires_live(self):
        a = PKV.BlockAllocator(2)
        with pytest.raises(ValueError):
            a.set_cacheable(0)

    def test_reset_clears_sharing_state(self):
        a = PKV.BlockAllocator(2)
        [b] = a.alloc(1)
        a.set_cacheable(b)
        a.share(b)
        a.reset()
        assert a.free_count == 2 and a.cached_count == 0
        assert a.refcount(b) == 0


class TestAllocatorChurn:
    """Seeded random-interleaving sweep over the allocator lifecycle —
    the op mix the growth engine produces (incremental grow, preemption
    bursts freeing whole maps, prefix shares, cacheable parking, and
    allocation-under-pressure eviction).  A shadow model tracks every
    expected refcount; after every op the allocator's FREE/LIVE/CACHED
    accounting must match the model *exactly*."""

    N_BLOCKS = 24

    def _check(self, a, ref, cached):
        """Compare allocator counters/refcounts against the shadow."""
        assert a.live_count == len(ref)
        assert a.cached_count == len(cached)
        assert a.free_count == self.N_BLOCKS - len(ref) - len(cached)
        assert a.available == a.free_count + a.cached_count
        assert set(ref) & cached == set()          # states are disjoint
        for b, rc in ref.items():
            assert a.refcount(b) == rc
        for b in cached:
            assert a.refcount(b) == 0

    @pytest.mark.parametrize("seed", range(5))
    def test_random_grow_free_preempt_evict_interleavings(self, seed):
        rng = np.random.default_rng(seed)
        evicted = []
        a = PKV.BlockAllocator(self.N_BLOCKS, on_evict=evicted.append)
        ref = {}                    # block -> expected refcount
        cached = set()              # expected CACHED set
        cacheable = set()           # marked set_cacheable while LIVE
        maps = []                   # request-style block lists (grow/free)
        peak = 0
        for _ in range(400):
            op = rng.choice(["admit", "grow", "share", "cacheable",
                             "preempt", "pressure"])
            if op == "admit" and a.can_alloc(2):
                blks = a.alloc(2)   # prompt-sized admission
                for b in blks:
                    assert ref.get(b, 0) == 0, "double alloc of live"
                    ref[b] = 1
                    cached.discard(b)
                    cacheable.discard(b)
                maps.append(list(blks))
            elif op == "grow" and maps and a.can_alloc(1):
                m = maps[rng.integers(len(maps))]
                [b] = a.alloc(1)    # one-block boundary crossing
                assert ref.get(b, 0) == 0
                ref[b] = 1
                cached.discard(b)
                cacheable.discard(b)
                m.append(b)
            elif op == "share" and maps:
                # prefix hit: pin one mapped block into another map
                m = maps[rng.integers(len(maps))]
                b = m[rng.integers(len(m))]
                a.share(b)
                ref[b] += 1
                maps.append([b])
            elif op == "cacheable" and maps:
                m = maps[rng.integers(len(maps))]
                b = m[rng.integers(len(m))]
                a.set_cacheable(b)
                cacheable.add(b)
            elif op == "preempt" and maps:
                # preemption/retire: decref a whole map at once
                m = maps.pop(rng.integers(len(maps)))
                a.free(m)
                for b in m:
                    ref[b] -= 1
                    if ref[b] == 0:
                        del ref[b]
                        if b in cacheable:
                            cached.add(b)
                        else:
                            cacheable.discard(b)
            elif op == "pressure":
                # allocate everything allocatable: forces LRU eviction
                # of every CACHED block, never touches LIVE ones
                n = a.available
                if n:
                    before = set(cached)
                    blks = a.alloc(n)
                    for b in blks:
                        assert ref.get(b, 0) == 0
                        ref[b] = 1
                    assert before <= set(blks)     # cached all recycled
                    cached.clear()
                    cacheable -= before
                    maps.append(list(blks))
            self._check(a, ref, cached)
            peak = max(peak, len(ref))
            assert a.peak_live >= len(ref)
        assert a.peak_live == peak
        # full teardown: every map released → pool returns to all-free
        # (+ whatever parked CACHED), then pressure drains CACHED too
        for m in maps:
            a.free(m)
            for b in m:
                ref[b] -= 1
                if ref[b] == 0:
                    del ref[b]
                    if b in cacheable:
                        cached.add(b)
                    else:
                        cacheable.discard(b)
        maps.clear()
        self._check(a, ref, cached)
        assert not ref
        assert a.free_count + a.cached_count == self.N_BLOCKS
        if a.available:
            a.alloc(a.available)               # evicts all CACHED
        assert a.live_count == self.N_BLOCKS   # exact accounting ✓


class TestPrefixIndex:
    def test_chain_hashes_full_blocks_only(self):
        idx = PKV.PrefixIndex(4, salt="s")
        assert len(idx.chain_hashes([1, 2, 3])) == 0
        assert len(idx.chain_hashes([1, 2, 3, 4])) == 1
        assert len(idx.chain_hashes(list(range(11)))) == 2

    def test_chain_binds_whole_prefix(self):
        """Block 1's hash differs when block 0's tokens differ — a match
        can never skip a mismatched earlier block."""
        idx = PKV.PrefixIndex(2, salt="s")
        h_ab = idx.chain_hashes([1, 2, 3, 4])
        h_cb = idx.chain_hashes([9, 9, 3, 4])
        assert h_ab[0] != h_cb[0] and h_ab[1] != h_cb[1]

    def test_salt_separates_configurations(self):
        """Same tokens under different format/layer salts never collide."""
        a = PKV.PrefixIndex(2, salt="kv8|L4")
        b = PKV.PrefixIndex(2, salt="kv4|L4")
        assert a.chain_hashes([1, 2]) != b.chain_hashes([1, 2])

    def test_match_walks_chain_and_stops_at_miss(self):
        idx = PKV.PrefixIndex(2, salt="s")
        h = idx.chain_hashes([1, 2, 3, 4, 5, 6])
        assert idx.register(h[0], 10) and idx.register(h[2], 12)
        # h[1] missing: match must stop after the first block even though
        # a deeper chain entry exists
        assert idx.match([1, 2, 3, 4, 5, 6]) == [10]
        assert idx.register(h[1], 11)
        assert idx.match([1, 2, 3, 4, 5, 6]) == [10, 11, 12]
        assert idx.match([1, 2, 9, 9]) == [10]     # diverging tokens

    def test_register_first_writer_wins(self):
        idx = PKV.PrefixIndex(2, salt="s")
        [h] = idx.chain_hashes([1, 2])
        assert idx.register(h, 5)
        assert not idx.register(h, 6)              # duplicate stays private
        assert idx.match([1, 2]) == [5]
        [h2] = idx.chain_hashes([3, 4])
        assert not idx.register(h2, 5)             # block already published

    def test_drop_block_idempotent(self):
        idx = PKV.PrefixIndex(2, salt="s")
        [h] = idx.chain_hashes([1, 2])
        idx.register(h, 5)
        idx.drop_block(5)
        assert idx.match([1, 2]) == [] and len(idx) == 0
        idx.drop_block(5)                          # no-op, no raise

    def test_allocator_eviction_drops_index_entry(self):
        """End-to-end retention loop: register → free to CACHED →
        eviction under pressure unpublishes the hash."""
        idx = PKV.PrefixIndex(2, salt="s")
        a = PKV.BlockAllocator(2, on_evict=idx.drop_block)
        [b] = a.alloc(1)
        [h] = idx.chain_hashes([1, 2])
        idx.register(h, b)
        a.set_cacheable(b)
        a.free([b])
        assert idx.match([1, 2]) == [b]
        a.alloc(2)                                 # forces eviction of b
        assert idx.match([1, 2]) == []


# ---------------------------------------------------------------------------
# COW block copy + slot gather (device halves of prefix sharing)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_copy_block_bitwise(key, fmt):
    """copy_block duplicates one pool block's bytes exactly and leaves
    every other block untouched."""
    spec, _, paged = _paired_caches(fmt, B=2, H=2, D=16, bs=4, max_seq=16)
    k = jax.random.normal(key, (2, 6, 2, 16), jnp.float32) \
        .astype(jnp.bfloat16)
    paged = PKV.append_paged(paged, k, -k, jnp.zeros((2,), jnp.int32), spec)
    src = int(paged.block_table[0, 0])
    dst = int(paged.block_table[1, 3])             # unwritten block
    out = PKV.copy_block(paged, jnp.int32(src), jnp.int32(dst))
    for leaf in ("k", "v", "k_scale", "v_scale"):
        a = np.asarray(getattr(paged, leaf))
        b = np.asarray(getattr(out, leaf))
        np.testing.assert_array_equal(b[dst], a[src], err_msg=leaf)
        mask = np.ones(a.shape[0], bool)
        mask[dst] = False
        np.testing.assert_array_equal(b[mask], a[mask], err_msg=leaf)


# ---------------------------------------------------------------------------
# Paged vs dense equivalence (per-format, ragged positions)
# ---------------------------------------------------------------------------


def _paired_caches(fmt, B=3, H=2, D=16, bs=4, max_seq=16, n_blocks=None):
    """Dense cache + paged cache with freshly allocated per-slot tables."""
    spec = _spec(fmt)
    bps = max_seq // bs
    n_blocks = n_blocks if n_blocks is not None else B * bps
    dense = KV.init_cache(B, max_seq, H, D, spec)
    paged = PKV.init_paged(B, n_blocks, bs, H, D, spec, blocks_per_slot=bps)
    alloc = PKV.BlockAllocator(n_blocks)
    tbl = paged.block_table
    for b in range(B):
        tbl = tbl.at[b, :bps].set(jnp.asarray(alloc.alloc(bps), jnp.int32))
    return spec, dense, dataclasses.replace(paged, block_table=tbl)


@pytest.mark.parametrize("fmt", ["kv16", "kv8", "kv4", "kvfp8"])
def test_append_read_matches_dense(key, fmt):
    """Interleaved ragged appends: every written position of the gathered
    paged view is bit-identical to the dense append_per_slot path."""
    B, H, D = 3, 2, 16
    spec, dense, paged = _paired_caches(fmt, B=B, H=H, D=D)
    pos = jnp.array([0, 3, 7], jnp.int32)
    written = [0, 3, 7]
    for step, T in enumerate((2, 1, 3)):           # varying chunk sizes
        k = jax.random.normal(jax.random.fold_in(key, 2 * step),
                              (B, T, H, D), jnp.float32) \
            .astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2 * step + 1),
                              (B, T, H, D), jnp.float32) \
            .astype(jnp.bfloat16)
        dense = KV.append_per_slot(dense, k, v, pos, spec)
        paged = PKV.append_paged(paged, k, v, pos, spec)
        pos = pos + T
        written = [w + T for w in written]

    view = PKV.gather_view(paged)
    assert view.k.shape == dense.k.shape
    np.testing.assert_array_equal(np.asarray(view.length),
                                  np.asarray(dense.length))
    for b in range(B):
        lo, hi = [0, 3, 7][b], written[b]
        for leaf in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(getattr(view, leaf)[b, lo:hi]),
                np.asarray(getattr(dense, leaf)[b, lo:hi]),
                err_msg=f"{fmt} slot {b} leaf {leaf}")


@pytest.mark.parametrize("fmt", ["kv16", "kv8", "kv4"])
def test_valid_masked_append_drops_padded_rows(key, fmt):
    """``append_paged(valid=...)``/``append_per_slot(valid=...)``: rows
    past a slot's valid count must leave the store untouched (padded
    mixed-step rows would otherwise dirty live cells of refcounted
    shared blocks), while valid rows land bit-identical to an unmasked
    append of the same tokens."""
    B, T, H, D = 3, 4, 2, 16
    spec, dense, paged = _paired_caches(fmt, B=B, H=H, D=D)
    pos = jnp.array([0, 3, 7], jnp.int32)
    valid = jnp.array([4, 1, 2], jnp.int32)
    k = jax.random.normal(key, (B, T, H, D), jnp.float32) \
        .astype(jnp.bfloat16)
    v = -k
    out_p = PKV.append_paged(paged, k, v, pos, spec, valid=valid)
    out_d = KV.append_per_slot(dense, k, v, pos, spec, valid=valid)
    # reference: per-slot unmasked appends of only the valid rows
    view = PKV.gather_view(out_p)
    for b in range(B):
        n = int(valid[b])
        ref = KV.append_per_slot(
            dense, k[:, :n], v[:, :n], pos, spec)
        for got in (view, out_d):
            np.testing.assert_array_equal(
                np.asarray(got.k[b, int(pos[b]):int(pos[b]) + n]),
                np.asarray(ref.k[b, int(pos[b]):int(pos[b]) + n]),
                err_msg=f"{fmt} slot {b} valid rows")
        # cells past the valid frontier stay at their init bytes
        np.testing.assert_array_equal(
            np.asarray(out_d.k[b, int(pos[b]) + n:]),
            np.asarray(dense.k[b, int(pos[b]) + n:]),
            err_msg=f"{fmt} slot {b} padded rows (dense)")
        np.testing.assert_array_equal(
            np.asarray(view.k[b, int(pos[b]) + n:]),
            np.asarray(PKV.gather_view(paged).k[b, int(pos[b]) + n:]),
            err_msg=f"{fmt} slot {b} padded rows (paged)")


def test_unmapped_writes_dropped(key):
    """Appends through sentinel table entries leave the pool untouched
    (a freed slot can never corrupt another slot's blocks)."""
    spec = _spec("kv8")
    paged = PKV.init_paged(2, 4, 4, 2, 8, spec, blocks_per_slot=2)
    # slot 0 mapped, slot 1 left at the sentinel
    paged = dataclasses.replace(
        paged, block_table=paged.block_table.at[0, :].set(
            jnp.array([1, 2], jnp.int32)))
    before = np.asarray(paged.k).copy()
    k = jax.random.normal(key, (2, 2, 2, 8), jnp.float32) \
        .astype(jnp.bfloat16)
    paged2 = PKV.append_paged(paged, k, k, jnp.array([0, 0], jnp.int32),
                              spec)
    after = np.asarray(paged2.k)
    # blocks 1-2 changed (slot 0's write), 0 and 3 untouched by slot 1
    assert not np.array_equal(after[1], before[1])
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[3], before[3])


def test_out_of_table_positions_dropped(key):
    """Positions beyond blocks_per_slot * block_size are dropped, not
    wrapped into other blocks."""
    spec = _spec("kv8")
    paged = PKV.init_paged(1, 2, 4, 1, 8, spec, blocks_per_slot=1)
    paged = dataclasses.replace(
        paged, block_table=paged.block_table.at[0, 0].set(0))
    before = np.asarray(paged.k).copy()
    k = jax.random.normal(key, (1, 2, 1, 8), jnp.float32) \
        .astype(jnp.bfloat16)
    # positions 6, 7 — outside the single mapped block's [0, 4) range
    paged2 = PKV.append_paged(paged, k, k, jnp.array([6], jnp.int32), spec)
    np.testing.assert_array_equal(np.asarray(paged2.k), before)


# ---------------------------------------------------------------------------
# Paged Pallas decode kernel (in-kernel block-table indirection; the full
# sweep lives in tests/test_kernels_paged_attn.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_paged_pallas_decode_matches_fused(key, fmt):
    """kernels/ops.kvattn_decode_paged ≈ the fused XLA path on a gathered
    dense view (interpret mode on CPU) — the kernel itself never
    gathers."""
    from repro.core import attention as A
    from repro.kernels import ops as kops

    B, H, D, bs, max_seq = 2, 2, 16, 8, 16
    spec, dense, paged = _paired_caches(fmt, B=B, H=H, D=D, bs=bs,
                                        max_seq=max_seq)
    pos = jnp.array([5, 5], jnp.int32)
    k = jax.random.normal(key, (B, 6, H, D), jnp.float32) \
        .astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, 6, H, D),
                          jnp.float32).astype(jnp.bfloat16)
    paged = PKV.append_paged(paged, k, v, jnp.zeros((B,), jnp.int32), spec)

    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, H, D),
                          jnp.float32).astype(jnp.bfloat16)
    # per-slot ragged positions — the shape the continuous-batching
    # engine's decode actually produces
    ragged = jnp.array([5, 3], jnp.int32)
    for p in (jnp.int32(5), ragged):
        out_pallas = kops.kvattn_decode_paged(q, paged, spec, p)
        out_fused = A.decode_attention(q, PKV.gather_view(paged), spec,
                                       p, impl="fused")
        np.testing.assert_allclose(
            np.asarray(out_pallas, np.float32),
            np.asarray(out_fused, np.float32), atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# Stacked pool: one layer addressed at a time, written in place
# ---------------------------------------------------------------------------


def _stacked(key, fmt, L=3, B=4, H=2, D=16, bs=4, max_seq=16):
    """A stacked pool whose layers hold different contents: every slot's
    first 10 positions written, layer by layer, with layer-specific KV;
    returned with each layer's dense twin.  The table maps slots 0-2
    (pool blocks shuffled) and leaves slot 3 and slot 2's last block at
    the sentinel."""
    spec = _spec(fmt)
    bps = max_seq // bs
    n_blocks = B * bps
    pool = PKV.init_paged(B, n_blocks, bs, H, D, spec, blocks_per_slot=bps,
                          n_layers=L)
    order = np.random.default_rng(11).permutation(n_blocks)
    tbl = np.full((B, bps), n_blocks, np.int32)
    tbl[:3] = order[:3 * bps].reshape(3, bps)
    tbl[2, -1] = n_blocks
    pool = dataclasses.replace(pool, block_table=jnp.asarray(tbl))
    dense = []
    for layer in range(L):
        kk = jax.random.fold_in(key, layer)
        k = jax.random.normal(kk, (B, 10, H, D), jnp.float32) \
            .astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(kk, 1), (B, 10, H, D),
                              jnp.float32).astype(jnp.bfloat16)
        zero = jnp.zeros((B,), jnp.int32)
        pool = PKV.append_paged(
            dataclasses.replace(pool, layer=jnp.int32(layer)), k, v, zero,
            spec)
        dense.append(KV.append_per_slot(KV.init_cache(B, max_seq, H, D, spec),
                                        k, v, zero, spec))
    return spec, pool, dense


def _expected_rows(pool, layer, kq, ks, vq, vs, pos, valid):
    """The pool leaves after an append at ``layer``, built row by row in
    numpy: each written (slot, row) lands at its block's row; rows past
    ``valid``, past the table, or through a sentinel land nowhere."""
    out = {n: np.array(getattr(pool, n)) for n in ("k", "v", "k_scale",
                                                  "v_scale")}
    tbl = np.asarray(pool.block_table)
    bs, bps, nb = pool.block_size, pool.blocks_per_slot, pool.n_blocks
    for b in range(tbl.shape[0]):
        for t in range(int(valid[b])):
            p = int(pos[b]) + t
            if p // bs >= bps or tbl[b, p // bs] >= nb:
                continue
            blk, r = tbl[b, p // bs], p % bs
            kvl = kq.shape[2] * kq.shape[3]
            heads = np.arange(kq.shape[2]) * bs + r
            out["k"][layer, blk, r, :kvl] = np.asarray(kq[b, t]).reshape(-1)
            out["v"][layer, blk, r, :kvl] = np.asarray(vq[b, t]).reshape(-1)
            out["k_scale"][layer, blk, 0, heads] = np.asarray(ks[b, t, :, 0])
            out["v_scale"][layer, blk, 0, heads] = np.asarray(vs[b, t, :, 0])
    return out


@pytest.mark.parametrize("fmt", ["kv16", "kv8", "kv4", "kvfp8"])
@pytest.mark.parametrize("T", [1, 6])
def test_stacked_append_writes_only_addressed_rows(key, fmt, T):
    """An append at layer 1 of a stacked pool changes exactly the rows it
    addresses in layer 1, bitwise, and no other byte: ragged positions
    whose chunk spans block edges, a slot cut short by ``valid``, an idle
    slot between writing ones (its grid steps repeat a neighbour's
    block), positions through a sentinel entry and past the table."""
    from repro.core import quantize as Q
    spec, pool, _ = _stacked(key, fmt)
    B, H, D = pool.n_slots, pool.kv_heads, 16
    k = jax.random.normal(jax.random.fold_in(key, 99), (B, T, H, D),
                          jnp.float32).astype(jnp.bfloat16)
    v = -k
    pos = jnp.array([3, 10, 11, 0], jnp.int32)
    valid = jnp.array([T, 0, T, T], jnp.int32)     # slot 1 idle
    if T > 1:
        valid = valid.at[0].set(T - 2)               # cut short
    out = PKV.append_paged(dataclasses.replace(pool, layer=jnp.int32(1)),
                           k, v, pos, spec, valid=valid)
    kq, ks = Q.quantize_kv(k, spec)
    vq, vs = Q.quantize_kv(v, spec)
    want = _expected_rows(pool, 1, kq, ks.astype(jnp.float32), vq,
                          vs.astype(jnp.float32), pos, valid)
    for leaf, arr in want.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(out, leaf)).view(np.uint8),
            arr.view(np.uint8), err_msg=f"{fmt} T={T} {leaf}")


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_stacked_append_with_nothing_to_write_changes_nothing(key, fmt):
    """Every slot idle (``valid == 0``), or every row through a sentinel:
    the writer still visits a block, and must write it back as it was."""
    spec, pool, _ = _stacked(key, fmt)
    B, H = pool.n_slots, pool.kv_heads
    k = jnp.ones((B, 3, H, 16), jnp.bfloat16)
    before = {n: np.asarray(getattr(pool, n)).copy()
              for n in ("k", "v", "k_scale", "v_scale")}
    # all idle; then slots 0-1 past the table, 2-3 through sentinels
    for valid, pos in ((jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)),
                       (None, jnp.array([16, 16, 12, 0], jnp.int32))):
        out = PKV.append_paged(
            dataclasses.replace(pool, layer=jnp.int32(2)), k, k, pos, spec,
            valid=valid)
        for leaf, arr in before.items():
            np.testing.assert_array_equal(
                np.asarray(getattr(out, leaf)), arr, err_msg=leaf)


@pytest.mark.parametrize("fmt", ["kv16", "kv8", "kv4", "kvfp8"])
def test_stacked_gather_view_matches_dense_per_layer(key, fmt):
    """``gather_view`` at each layer of a stacked pool equals, at every
    written position, what the dense ``append_per_slot`` stored for that
    layer — the layers' contents differ, so a wrong layer shows."""
    spec, pool, dense = _stacked(key, fmt)
    for layer, d in enumerate(dense):
        view = PKV.gather_view(dataclasses.replace(pool,
                                                   layer=jnp.int32(layer)))
        for b in range(3):      # the mapped slots; positions 0-9 written
            for leaf in ("k", "v", "k_scale", "v_scale"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(view, leaf)[b, :10]),
                    np.asarray(getattr(d, leaf)[b, :10]),
                    err_msg=f"{fmt} layer {layer} slot {b} {leaf}")
