"""Engine-level prefix-sharing guarantees (DESIGN.md §5.2).

* **Byte-identity**: greedy streams with ``enable_prefix_caching`` must
  be byte-identical to the sharing-disabled paged engine — shared blocks
  hold the exact bytes a cold prefill would have produced, and the kernel
  reads the same pool tiles either way.
* **Single allocation**: requests sharing a block-aligned prompt prefix
  map the *same physical blocks* (refcounted), never duplicates.
* **Lifecycle**: abort/retire decref instead of free; eviction under pool
  pressure unpublishes prefixes without corrupting live requests.
"""
import pytest

from repro.configs import get_reduced
from repro.serving import Engine, EngineConfig, EngineError, SamplingParams

BS = 8
SYS = list(range(1, 18))        # 17-token "system prompt": 2 full blocks


def _mk_engine(prefix_caching=True, **kw):
    args = dict(n_slots=3, max_seq=64, max_prompt=32, seed=0,
                cache_kind="paged", block_size=BS, prefill_chunk=4,
                enable_prefix_caching=prefix_caching)
    args.update(kw)
    return Engine(EngineConfig(model=get_reduced("smollm-360m"),
                               policy="w4a16kv8", **args))


def _drain(eng):
    return {o.rid: o for o in eng.run_until_idle()}


def _greedy(eng, prompts, max_new=5):
    rids = [eng.submit(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]
    final = _drain(eng)
    return [final[r] for r in rids]


def test_dense_engine_rejects_prefix_caching():
    with pytest.raises(EngineError, match="prefix_caching"):
        EngineConfig(model=get_reduced("smollm-360m"), cache_kind="dense",
                     enable_prefix_caching=True)


class TestByteIdentity:
    @pytest.fixture(scope="class")
    def engines(self):
        return _mk_engine(False), _mk_engine(True)

    def test_prefix_hit_streams_identical_to_cold(self, engines):
        """Donor request registers the prefix; later requests hit it.
        Every stream (donor, hits, and a block-aligned COW-tail prompt)
        must match the sharing-disabled engine byte for byte."""
        prompts = ([SYS + [100 + i] for i in range(4)]
                   + [SYS[:2 * BS]]          # block-aligned: COW tail
                   + [[7, 7] + SYS])         # diverging first block: miss
        streams, cached = [], []
        for eng in engines:
            outs = _greedy(eng, prompts)
            streams.append([o.output_token_ids for o in outs])
            cached.append([o.cached_tokens for o in outs])
        assert streams[0] == streams[1], "prefix sharing changed tokens"
        assert cached[0] == [0] * len(prompts)   # disabled: never cached
        # 3 slots: the first 3 requests admit together before any
        # registration; the 4th hits both full blocks, the 5th COWs the
        # block holding its last prompt token, the 6th diverges (miss)
        assert cached[1][3] == 2 * BS
        assert cached[1][4] == 2 * BS - 1        # last token is re-decoded
        assert cached[1][5] == 0

    def test_hits_across_generations(self, engines):
        """Blocks cached by *retired* requests (refcount 0, CACHED state)
        still serve hits, and streams still match the cold engine."""
        prompts = [SYS + [60], SYS + [61]]
        streams = []
        for eng in engines:
            outs = _greedy(eng, prompts)
            streams.append([o.output_token_ids for o in outs])
        assert streams[0] == streams[1]

    def test_streaming_surface_identical(self, engines):
        cold, warm = engines
        toks = []
        for eng in (cold, warm):
            got = []
            for out in eng.stream(SYS + [77],
                                  SamplingParams(max_new_tokens=6)):
                got.extend(out.new_token_ids)
            toks.append(got)
        assert toks[0] == toks[1] and len(toks[0]) == 6


class TestAllocatorAccounting:
    def test_shared_blocks_allocated_once(self):
        """Two concurrent requests sharing a 2-block prefix hold the same
        two physical blocks at refcount 2 — the pool pays for the shared
        prefix exactly once."""
        eng = _mk_engine(True)
        donor = eng.submit(SYS, SamplingParams(max_new_tokens=2))
        _drain(eng)
        a = eng.submit(SYS + [101], SamplingParams(max_new_tokens=4))
        b = eng.submit(SYS + [102], SamplingParams(max_new_tokens=4))
        eng.step()                                 # admit + prefill both
        shared_a = eng._block_map[a][:2]
        shared_b = eng._block_map[b][:2]
        assert shared_a == shared_b                # same physical blocks
        assert [eng.allocator.refcount(blk) for blk in shared_a] == [2, 2]
        # pool accounting: worst case is 2 blocks per request total for
        # the shared prefix, not 2 + 2
        need = eng._blocks_for(eng._requests[a])
        assert len(set(eng._block_map[a]) | set(eng._block_map[b])) \
            == 2 * need - 2
        final = _drain(eng)
        assert final[a].cached_tokens == final[b].cached_tokens == 2 * BS
        # retirement decrefs to zero; published blocks park as CACHED
        assert eng.allocator.live_count == 0
        assert eng.allocator.cached_count >= 2
        assert eng.allocator.free_count + eng.allocator.cached_count \
            == eng.n_blocks

    def test_cow_source_keeps_other_sharers_intact(self):
        """A COW materialization copies — the source block's bytes keep
        serving other requests (and future hits) unchanged."""
        eng = _mk_engine(True)
        donor = SYS[:2 * BS] + [50]                # registers 2 blocks
        _greedy(eng, [donor])
        cow_out = _greedy(eng, [SYS[:2 * BS]])[0]  # COWs block 1
        assert cow_out.cached_tokens == 2 * BS - 1
        hit = _greedy(eng, [donor])[0]             # source chain intact
        assert hit.cached_tokens == 2 * BS
        cold = _greedy(_mk_engine(False), [donor])[0]
        assert hit.output_token_ids == cold.output_token_ids

    def test_abort_decrefs_shared_blocks(self):
        """Aborting one of two sharers releases only its references; the
        survivor keeps decoding on the still-live shared blocks."""
        eng = _mk_engine(True)
        _greedy(eng, [SYS])                        # register the prefix
        a = eng.submit(SYS + [101], SamplingParams(max_new_tokens=6))
        b = eng.submit(SYS + [102], SamplingParams(max_new_tokens=6))
        eng.step()
        shared = eng._block_map[a][:2]
        eng.abort(a)
        assert [eng.allocator.refcount(blk) for blk in shared] == [1, 1]
        out = _drain(eng)[b]
        assert len(out.output_token_ids) == 6
        assert eng.allocator.live_count == 0
        cold = _greedy(_mk_engine(False), [SYS + [102]], max_new=6)[0]
        assert out.output_token_ids == cold.output_token_ids

    def test_eviction_under_pressure_stays_correct(self):
        """A pool too small to retain every prefix evicts LRU cached
        blocks for new allocations; evicted prefixes simply miss (cold
        prefill) and streams stay byte-identical to a cold engine."""
        eng = _mk_engine(True, n_slots=2, n_blocks=6, max_seq=32)
        cold = _mk_engine(False, n_slots=2, n_blocks=6, max_seq=32)
        prompts = [[i + 1] * 9 + [i + 2] * 8 for i in range(5)]
        warm_outs = [_greedy(eng, [p], max_new=3)[0] for p in prompts]
        cold_outs = [_greedy(cold, [p], max_new=3)[0] for p in prompts]
        assert [o.output_token_ids for o in warm_outs] \
            == [o.output_token_ids for o in cold_outs]
        # the allocator never leaked: every block is free or cached
        assert eng.allocator.live_count == 0
        assert eng.allocator.free_count + eng.allocator.cached_count == 6

    def test_cow_pin_degrades_instead_of_livelock(self):
        """The COW source pin needs one transient extra block; in a pool
        sized exactly to the request's worst case that +1 can never fit,
        so the gate must degrade the tail to a recomputed miss — not
        defer forever a request the unshared engine admits at once."""
        streams = []
        for prefix in (True, False):
            eng = _mk_engine(prefix, n_slots=2, n_blocks=3, max_seq=32)
            _greedy(eng, [SYS], max_new=2)         # donor: 2 blocks cached
            a = eng.submit(SYS[:16], SamplingParams(max_new_tokens=9))
            eng.step()
            assert len(eng.scheduler.running()) == 1   # admitted, no defer
            out = _drain(eng)[a]
            streams.append(out.output_token_ids)
            if prefix:
                assert out.cached_tokens == BS     # degraded: RO hit only
        assert streams[0] == streams[1]

    def test_full_hit_keeps_length_invariant(self):
        """A full prefix hit stages nothing, but the slot's advisory
        ``length`` must still cover the decode frontier — live_ctx's
        "length >= every true frontier" over-estimate contract."""
        import numpy as np
        eng = _mk_engine(True, n_slots=1)
        _greedy(eng, [SYS], max_new=2)             # registers 2 blocks
        _greedy(eng, [[5, 6]], max_new=2)          # slot length drops low
        a = eng.submit(SYS, SamplingParams(max_new_tokens=2))
        eng.step()                                 # full hit: skip = 16
        assert eng._requests[a].prefix_skip == len(SYS) - 1
        assert int(np.asarray(eng.cache.length)[0]) >= len(SYS) - 1
        _drain(eng)

    def test_admission_with_hits_beats_cold_capacity(self):
        """Reserving only non-shared blocks admits requests a cold pool
        could not: in a 5-block pool, two 18-token-prompt requests
        (3 blocks worst case each) run concurrently only because the
        2-block prefix is shared — the sharing-disabled engine defers
        the second request."""
        ps = SamplingParams(max_new_tokens=4)
        cold = _mk_engine(False, n_slots=3, n_blocks=5, max_seq=32)
        cold.submit(SYS + [9], ps)
        cold.submit(SYS + [8], ps)
        cold.step()
        assert len(cold.scheduler.running()) == 1  # 3+3 > 5: deferred

        eng = _mk_engine(True, n_slots=3, n_blocks=5, max_seq=32)
        _greedy(eng, [SYS], max_new=2)             # register 2 blocks
        assert eng.allocator.cached_count == 2
        a, b = eng.submit(SYS + [9], ps), eng.submit(SYS + [8], ps)
        eng.step()
        # worst case each: 18+4-1=21 tokens → 3 blocks; the shared
        # prefix covers 2, so both fit in 2*3-2=4 live blocks of 5
        assert len(eng.scheduler.running()) == 2
        assert eng.allocator.live_count == 4
        final = _drain(eng)
        assert final[a].cached_tokens == final[b].cached_tokens == 16
